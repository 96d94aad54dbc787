"""The latent-attention layer's layout contract (doc/sequence.md, PR 35):
``q``, ``k``, ``v`` and ``o`` are made head-major, ``(batch, heads, seq,
dim)``, by the products that make them; the parameters stay as published.
Held here against the plain ``(batch, seq, heads, dim)`` spelling the layer
had before, kept in this file, and against a model file that layer wrote."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import ForwardContext, NodeSpec
from cxxnet_tpu.layers.sequence import LatentAttentionLayer, rms_norm
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.ops.attention import causal_attention
from cxxnet_tpu.utils.config import parse_config_string

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'fixtures')
D, SEQ = 48, 32
WIDTHS = dict(nhead=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
              qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000,
              init_sigma=0.2)
LEAVES = LatentAttentionLayer.param_fields


def _layer():
    layer = LatentAttentionLayer('attn')
    for key, val in WIDTHS.items():
        layer.set_param(key, str(val))
    layer.infer_shapes([NodeSpec(D, 1, SEQ)])
    return layer


def _plain_rope(x, theta):
    """Rotary over ``(..., seq, heads, dim)``, half-split pairs."""
    seq, dim = x.shape[-3], x.shape[-1]
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _plain_forward(layer, params, inputs, ctx):
    """The layer as it was written before PR 35: ``(seq, heads * dim)``
    products, then slices, concats and transposes to the kernel's operands
    and back.  Same rounding points."""
    h = inputs[0][:, 0]
    b, s, _ = h.shape
    dt, nh = h.dtype, layer.nhead
    nope, rd, vd, kvr = (layer.nope, layer.rope_dim, layer.v_dim,
                         layer.kv_lora_rank)
    dot = lambda a, w: jnp.dot(                          # noqa: E731
        a, w.astype(dt), preferred_element_type=jnp.float32).astype(dt)
    x = rms_norm(h, params['norm'], layer.eps)
    c_q = rms_norm(dot(x, params['wq_a']), params['q_norm'], layer.eps)
    q = dot(c_q, params['wq_b']).reshape(b, s, nh, nope + rd)
    ckv = dot(x, params['wkv_a'])
    c_kv, k_r = ckv[..., :kvr], ckv[..., kvr:]
    kv = dot(rms_norm(c_kv, params['kv_norm'], layer.eps),
             params['wkv_b']).reshape(b, s, nh, nope + vd)
    q_rope = _plain_rope(q[..., nope:], layer.rope_theta)
    k_rope = _plain_rope(k_r[:, :, None, :], layer.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, nh, rd))], axis=-1)
    v = kv[..., nope:]
    o = causal_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3),
                         1.0 / math.sqrt(nope + rd), ctx.spmd_devices)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * vd)
    out = jnp.dot(o, params['wo'].astype(dt),
                  preferred_element_type=jnp.float32)
    return [(h.astype(jnp.float32) + out).astype(dt)[:, None]]


@pytest.fixture(scope='module')
def both():
    """(dtype, batch) -> output, ``dh`` and the eight leaves' gradients of
    the layer and of the plain spelling, on the same seeded arrays; made on
    first use."""
    layer, ctx, made = _layer(), ForwardContext(is_train=True), {}

    def make(dtype, batch):
        if (dtype, batch) not in made:
            keys = jax.random.split(jax.random.PRNGKey(7 + batch), 4)
            params = layer.init_params(keys[0], [NodeSpec(D, 1, SEQ)])
            for i, name in enumerate(('norm', 'q_norm', 'kv_norm')):
                params[name] = 1.0 + 0.3 * jax.random.normal(
                    jax.random.fold_in(keys[1], i), params[name].shape)
            h = jax.random.normal(keys[2], (batch, 1, SEQ, D)).astype(dtype)
            g = jax.random.normal(keys[3], h.shape)

            def run(forward):
                def loss(p, h):
                    out = forward(p, [h], ctx)[0]
                    return jnp.sum(out.astype(jnp.float32) * g), out
                (_, out), (dp, dh) = jax.value_and_grad(
                    loss, argnums=(0, 1), has_aux=True)(params, h)
                return dict(dp, output=out, dh=dh)

            made[dtype, batch] = (
                run(layer.forward),
                run(lambda p, x, c: _plain_forward(layer, p, x, c)))
        return made[dtype, batch]
    return make


@pytest.mark.parametrize('what', ('output', 'dh') + LEAVES)
@pytest.mark.parametrize('batch', [1, 2])
@pytest.mark.parametrize('dtype,tol', [('float32', 1e-5), ('bfloat16', 2e-2)])
def test_head_major_layer_equals_the_plain_spelling(both, dtype, tol, batch,
                                                    what):
    """Output, ``dh`` and every leaf's gradient: float32 to 1e-5 of the
    array's largest magnitude; bf16 (the same rounding points, another
    order of summation inside a product) to two of its steps."""
    got, want = (np.asarray(side[what], np.float32)
                 for side in both(dtype, batch))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(want).max() > 0
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def test_leaves_keep_their_names_and_published_shapes():
    layer = _layer()
    shapes = jax.eval_shape(lambda: layer.init_params(
        jax.random.PRNGKey(0), [NodeSpec(D, 1, SEQ)]))
    assert {k: v.shape for k, v in shapes.items()} == {
        'norm': (D,), 'wq_a': (D, 24), 'q_norm': (24,),
        'wq_b': (24, 4 * (12 + 8)), 'wkv_a': (D, 16 + 8),
        'kv_norm': (16,), 'wkv_b': (16, 4 * (12 + 16)),
        'wo': (4 * 16, D)}


# --- a model file the layer before PR 35 wrote --------------------------------
# tests/fixtures/mla_pr34.model: ``save_model`` of this conf at commit a5022f5
# (PR 34) after two updates; mla_pr34_probs.npy: that program's ``probs`` node
# on ``_ids()``.

MLA_CONF = """
netconfig = start
layer[0->ids] = seq_slice
  offset = 0
layer[ids->h] = embedding:emb
  nhidden = 32
  vocab_held = 24
  vocab_published = 24
layer[h->h] = mla:attn
layer[h->hn] = rmsnorm:final_norm
layer[hn->probs] = lm_head_loss:head
  vocab_held = 24
  vocab_published = 24
  target = label
  chunk_tokens = 8
netconfig = end
nhead = 2
q_lora_rank = 12
kv_lora_rank = 8
qk_nope_head_dim = 12
qk_rope_head_dim = 4
v_head_dim = 8
rope_theta = 10000
eps = 0.00001
seq_len = 16
input_shape = 1,1,17
label_vec[0,16) = label
batch_size = 2
dev = cpu
compute_type = float32
random_type = gaussian
init_sigma = 0.3
updater = adam
eta = 0.01
wd = 0.0
eval_train = 0
seed = 11
silent = 1
"""


def _ids():
    return (np.arange(2 * 17).reshape(2, 17) * 7 + 3) % 24


def _mla_batch():
    from cxxnet_tpu.io.data import DataBatch
    ids = _ids()
    return DataBatch(ids[:, None, None, :].astype(np.float32),
                     ids[:, 1:].astype(np.float32))


def test_a_model_file_of_the_layer_before_loads_with_the_same_forward():
    """The leaves' names and shapes did not change: a model file written by
    the parent's layer loads, leaf for leaf, and gives its probabilities."""
    tr = NetTrainer(parse_config_string(MLA_CONF))
    with open(os.path.join(FIXTURES, 'mla_pr34.model'), 'rb') as f:
        tr.load_model(f)
    fresh = NetTrainer(parse_config_string(MLA_CONF))
    fresh.init_model()
    attn = next(d for d in tr.params.values() if 'wq_b' in d)
    assert sorted(attn) == sorted(LEAVES)
    for mine, theirs in zip(jax.tree.leaves(tr.params),
                            jax.tree.leaves(fresh.params)):
        assert mine.shape == theirs.shape
    want = np.load(os.path.join(FIXTURES, 'mla_pr34_probs.npy'))
    got = tr.extract_feature(_mla_batch(), 'probs')
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
