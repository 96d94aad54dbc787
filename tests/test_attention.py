"""``ops/attention.py``, the one attention a training step runs: which path
``causal_attention`` takes from what it can observe (the backend, the mesh,
the shapes), what it hands JAX's flash kernel on the chip, and the XLA path
over checkpointed blocks of queries - the one every CPU run and every mesh
run of the language model takes - against a dense masked softmax, values and
gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops import attention
from cxxnet_tpu.ops.attention import causal_attention_xla


@pytest.mark.parametrize('dv', [16, 24])
def test_blocked_attention_equals_the_full_masked_softmax(dv):
    """The XLA path over blocks of queries (each recomputed in the backward
    pass) against one full masked softmax: values and all three gradients,
    value dims equal and unequal to the key dims."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 3, 64, 16))
    k = jax.random.normal(ks[1], (2, 3, 64, 16))
    v = jax.random.normal(ks[2], (2, 3, 64, dv))

    def loss(block):
        return lambda q, k, v: jnp.sum(jnp.sin(
            causal_attention_xla(q, k, v, 0.25, block_q=block)))
    full = jax.value_and_grad(loss(64), argnums=(0, 1, 2))(q, k, v)
    blocked = jax.value_and_grad(loss(16), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(blocked)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-5)
    # causal: the first position sees itself alone
    out = causal_attention_xla(q, k, v, 0.25, block_q=16)
    np.testing.assert_allclose(out[:, :, 0], v[:, :, 0], atol=1e-6)


# --- the gate: one row a decision ---------------------------------------------

def _qkv(seq=8192, dq=256, dk=None, dv=None, heads=20):
    s = lambda d: jax.ShapeDtypeStruct(  # noqa: E731
        (1, heads, seq, d), jnp.bfloat16)
    return s(dq), s(dq if dk is None else dk), s(dq if dv is None else dv)


GATE = {
    # the benchmark cell's attention (20 heads of 256 over 8,192), on its chip
    'tpu-20x8192x256': ('tpu', _qkv(), 1, True),
    # the same shapes in this sandbox: the kernel has no interpret mode
    'cpu': ('cpu', _qkv(), 1, False),
    # the kernel takes head dims that fill the 128 lanes
    'head-dim-192': ('tpu', _qkv(dq=192), 1, False),
    'head-dim-64': ('tpu', _qkv(dq=64), 1, False),
    # and sequences its smaller block divides
    'seq-8192+256': ('tpu', _qkv(seq=8192 + 256), 1, False),
    'seq-512': ('tpu', _qkv(seq=512), 1, True),
    # latent attention before its keys and values are padded to the queries'
    'k-narrower': ('tpu', _qkv(dk=128), 1, False),
    'v-narrower': ('tpu', _qkv(dv=128), 1, False),
    # a Mosaic call has no partitioning rule: a mesh takes the XLA path
    'mesh-of-2': ('tpu', _qkv(), 2, False),
    'mesh-of-4': ('tpu', _qkv(), 4, False),
}


@pytest.mark.parametrize('case', sorted(GATE))
def test_use_flash_reads_the_backend_and_the_shape(case, monkeypatch):
    backend, (q, k, v), spmd, want = GATE[case]
    monkeypatch.delenv('CXXNET_PALLAS', raising=False)
    monkeypatch.setattr(jax, 'default_backend', lambda: backend)
    assert attention._use_flash(q, k, v, spmd) is want


@pytest.mark.parametrize('seq,major', [(8192, 1024), (1536, 512)])
def test_flash_blocks_follow_the_sequence(seq, major, monkeypatch):
    """What ``_flash`` hands JAX's kernel: causal, the layer's scale, and
    the tiles measured on the v5e - 1024-row major blocks where they
    divide the sequence, 512 where only those do."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    heard = {}

    def recorder(q, k, v, **kw):
        heard.update(kw)
        return q

    monkeypatch.setattr(fa, 'flash_attention', recorder)
    q = jnp.zeros((1, 2, seq, 128), jnp.bfloat16)
    assert attention._flash(q, q, q, 0.125) is q
    assert heard['causal'] is True and heard['sm_scale'] == 0.125
    blocks = heard['block_sizes']
    assert blocks == fa.BlockSizes(
        block_q=major, block_k_major=major, block_k=512, block_b=1,
        block_q_major_dkv=major, block_k_major_dkv=major, block_k_dkv=512,
        block_q_dkv=512, block_k_major_dq=major, block_k_dq=512,
        block_q_dq=major)


# --- the XLA path against a dense masked softmax ------------------------------

def _dense(q, k, v, scale):
    """softmax(q k^T scale, causal) v with the whole score matrix, float32."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    n = scores.shape[-1]
    scores = jnp.where(jnp.tril(jnp.ones((n, n), bool)), scores, -jnp.inf)
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, -1), v)


def _operands(batch, heads, seq, dk, dv, dtype=jnp.float32, seed=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (batch, heads, seq, dk), dtype),
            jax.random.normal(ks[1], (batch, heads, seq, dk), dtype),
            jax.random.normal(ks[2], (batch, heads, seq, dv), dtype))


#: (what, (batch, heads, seq, dk, dv), block_q)
BLOCKED = {
    # forward: a length no block divides is one masked softmax ...
    'fwd-ragged-one-block': ('fwd', (1, 2, 100, 16, 16), 32),
    # ... and so is a length under a block
    'fwd-under-a-block': ('fwd', (1, 2, 24, 16, 16), 32),
    'fwd-batch2-heads3': ('fwd', (2, 3, 64, 16, 16), 16),
    # backward: through the jax.checkpoint inside lax.map
    'grad-4-blocks': ('grad', (1, 2, 64, 8, 8), 16),
    'grad-3-blocks-dv-wider': ('grad', (2, 3, 96, 16, 24), 32),
    'grad-2-blocks-dv-narrower': ('grad', (1, 1, 128, 32, 16), 64),
    'bf16-in-a-band': ('bf16', (1, 2, 64, 16, 16), 16),
}


@pytest.mark.parametrize('case', sorted(BLOCKED))
def test_blocked_attention_matches_the_dense_softmax(case):
    what, shape, block = BLOCKED[case]
    scale = shape[3] ** -0.5
    if what == 'bf16':
        q, k, v = _operands(*shape, dtype=jnp.bfloat16)
        out = causal_attention_xla(q, k, v, scale, block_q=block)
        assert out.dtype == jnp.bfloat16
        # scores and softmax in float32; the probabilities and the output
        # are rounded to bf16: 2**-8 relative, on values of order one
        np.testing.assert_allclose(out.astype(jnp.float32),
                                   _dense(q, k, v, scale), atol=3e-2)
        return
    q, k, v = _operands(*shape)
    if what == 'fwd':
        np.testing.assert_allclose(
            causal_attention_xla(q, k, v, scale, block_q=block),
            _dense(q, k, v, scale), atol=2e-5, rtol=1e-5)
        return
    weight = jnp.cos(jnp.arange(np.prod(shape[:3]) * shape[4],
                                dtype=jnp.float32)).reshape(
        shape[:3] + (shape[4],))
    got = jax.grad(lambda *a: jnp.sum(weight * causal_attention_xla(
        *a, scale, block_q=block)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(weight * _dense(*a, scale)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, 'qkv'):
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=1e-4,
                                   err_msg=f'd{name}')
