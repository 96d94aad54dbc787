"""Pallas kernel differential tests (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops.pallas_kernels import pallas_matmul


@pytest.mark.parametrize('m,k,n', [(100, 64, 70), (256, 512, 256)])
def test_pallas_matmul(m, k, n):
    rng = np.random.RandomState(3)
    a = rng.randn(m, k).astype(np.float32)
    b = rng.randn(k, n).astype(np.float32)
    out = np.asarray(pallas_matmul(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)


def test_clamp_tile():
    """Default tiles shrink to the covered dim (lane-aligned): fullc's
    production m=256 must not be padded to the TN kernel's old fixed
    tile_m=512 (that halved its throughput, receipts/micro_matmul_bwd)."""
    from cxxnet_tpu.ops.pallas_kernels import _clamp_tile
    assert _clamp_tile(512, 256) == 256
    assert _clamp_tile(512, 1000) == 512
    assert _clamp_tile(256, 100) == 128
    assert _clamp_tile(128, 8) == 128


def test_pallas_matmul_grad():
    rng = np.random.RandomState(5)
    a = jnp.asarray(rng.randn(64, 48).astype(np.float32))
    b = jnp.asarray(rng.randn(48, 32).astype(np.float32))
    g = jnp.asarray(rng.randn(64, 32).astype(np.float32))
    da, db = jax.vjp(pallas_matmul, a, b)[1](g)
    np.testing.assert_allclose(np.asarray(da), np.asarray(g @ b.T),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(a.T @ g),
                               rtol=1e-4, atol=1e-4)


class TestFlashAttention:
    def _rand(self, b, s, h, d, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        return mk(), mk(), mk()

    @pytest.mark.parametrize('causal', [False, True])
    def test_matches_reference(self, causal):
        from cxxnet_tpu.ops.pallas_kernels import flash_attention
        from cxxnet_tpu.parallel.sequence import attention_reference
        q, k, v = self._rand(2, 32, 2, 16)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    @pytest.mark.parametrize('causal', [False, True])
    def test_gradients_match(self, causal):
        from cxxnet_tpu.ops.pallas_kernels import flash_attention
        from cxxnet_tpu.parallel.sequence import attention_reference
        q, k, v = self._rand(1, 24, 2, 8, seed=1)

        def loss_f(f):
            return lambda q, k, v: jnp.sum(
                f(q, k, v) * jnp.cos(jnp.arange(q.size).reshape(q.shape)))

        f = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            block_q=8, block_k=8)
        r = lambda q, k, v: attention_reference(q, k, v, causal=causal)
        g = jax.grad(loss_f(f), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_f(r), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(a, b, atol=2e-5)

    def test_ragged_seq_padding(self):
        # seq not a multiple of the block: padded keys must not leak
        from cxxnet_tpu.ops.pallas_kernels import flash_attention
        from cxxnet_tpu.parallel.sequence import attention_reference
        q, k, v = self._rand(1, 21, 2, 8, seed=2)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_cross_attention_shapes(self):
        from cxxnet_tpu.ops.pallas_kernels import flash_attention
        from cxxnet_tpu.parallel.sequence import attention_reference
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(2, 12, 2, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 40, 2, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 40, 2, 8), jnp.float32)
        out = flash_attention(q, k, v, block_q=8, block_k=8)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_ulysses_flash_path(self, monkeypatch):
        from cxxnet_tpu.parallel.sequence import (attention_reference,
                                                  ulysses_attention)
        from jax.sharding import Mesh
        monkeypatch.setenv('CXXNET_PALLAS', '1')
        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ('data',))
        q, k, v = self._rand(2, 32, 4, 8, seed=4)
        out = ulysses_attention(q, k, v, mesh, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)


def test_attn_use_flash_gate(monkeypatch):
    """'auto' engages flash only on real TPU where the dense score
    matrix (batch*heads*seq^2 f32) blows the HBM budget; explicit on/off
    force both ways."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.delenv('CXXNET_PALLAS', raising=False)
    monkeypatch.setattr(pk, '_interpret', lambda: True)
    assert not pk.attn_use_flash(16384, batch=2, heads=8)
    monkeypatch.setattr(pk, '_interpret', lambda: False)
    assert pk.attn_use_flash(16384, batch=2, heads=8)    # ~17 GB
    assert pk.attn_use_flash(4096, batch=64, heads=16)   # big b*h
    assert not pk.attn_use_flash(4096, batch=2, heads=8)     # ~1 GB
    assert not pk.attn_use_flash(16384)                      # b1 h1: fits
    monkeypatch.setenv('CXXNET_PALLAS', '1')
    assert pk.attn_use_flash(64)
    monkeypatch.setenv('CXXNET_PALLAS', '0')
    assert not pk.attn_use_flash(16384, batch=2, heads=8)


def test_matmul_wide_n_preset_numerics():
    """The measured-winning fc6 tile preset (MATMUL_TILES_WIDE_N,
    BASELINE.md kernel table) must be numerically identical to the
    default tiling — it is a pure schedule change."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    rng = np.random.RandomState(6)
    a = jnp.asarray(rng.randn(64, 192).astype(np.float32))
    b = jnp.asarray(rng.randn(192, 96).astype(np.float32))
    out = pk._matmul_impl(a, b, *pk.MATMUL_TILES_WIDE_N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)
