"""Pallas kernel differential tests (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops.pallas_kernels import lrn_pallas, pallas_matmul


def lrn_ref(x, nsize, alpha, beta, knorm):
    """Pure-jnp LRN (the XLA path in layers/norm.py)."""
    c = x.shape[-1]
    half_lo = (nsize - 1) // 2
    sq = x * x
    out = np.zeros_like(x)
    for ch in range(c):
        lo = max(0, ch - half_lo)
        hi = min(c, ch + (nsize - 1 - half_lo) + 1)
        norm = knorm + alpha / nsize * np.sum(sq[..., lo:hi], axis=-1)
        out[..., ch] = x[..., ch] * norm ** -beta
    return out


@pytest.mark.parametrize('nsize', [3, 5, 4])
def test_lrn_pallas_forward(nsize):
    rng = np.random.RandomState(0)
    x = rng.rand(2, 3, 5, 96).astype(np.float32)
    out = np.asarray(lrn_pallas(jnp.asarray(x), nsize, 0.001, 0.75, 1.0))
    ref = lrn_ref(x, nsize, 0.001, 0.75, 1.0)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('nsize', [5, 4])
def test_lrn_pallas_grad_matches_autodiff(nsize):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.rand(2, 2, 3, 32).astype(np.float32) + 0.1)

    def jnp_lrn(x):
        c = x.shape[-1]
        half_lo = (nsize - 1) // 2
        half_hi = nsize - 1 - half_lo
        sq = x * x
        pad = jnp.pad(sq, [(0, 0)] * 3 + [(half_lo + 1, half_hi)])
        cums = jnp.cumsum(pad, axis=-1)
        win = cums[..., nsize:nsize + c] - cums[..., 0:c]
        norm = win * (0.001 / nsize) + 1.0
        return x * jnp.power(norm, -0.75)

    g_ref = jax.grad(lambda x: jnp.sum(jnp_lrn(x) ** 2))(x)
    g_pl = jax.grad(lambda x: jnp.sum(
        lrn_pallas(x, nsize, 0.001, 0.75, 1.0) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_pl), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize('nsize', [5, 4])
def test_lrn_hybrid_matches_full_pallas(nsize):
    """lrn_hybrid (pallas fwd / XLA bwd, the default TPU path at
    MXU-aligned channel counts) must agree with lrn_pallas in both
    passes."""
    from cxxnet_tpu.ops.pallas_kernels import lrn_hybrid
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.rand(2, 2, 3, 32).astype(np.float32) + 0.1)
    out_h = lrn_hybrid(x, nsize, 0.001, 0.75, 1.0)
    out_p = lrn_pallas(x, nsize, 0.001, 0.75, 1.0)
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(out_p),
                               rtol=1e-5, atol=1e-6)
    g_h = jax.grad(lambda x: jnp.sum(
        lrn_hybrid(x, nsize, 0.001, 0.75, 1.0) ** 2))(x)
    g_p = jax.grad(lambda x: jnp.sum(
        lrn_pallas(x, nsize, 0.001, 0.75, 1.0) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_h), np.asarray(g_p),
                               rtol=1e-4, atol=1e-5)


def test_lrn_auto_mode_gate(monkeypatch):
    """'auto' picks full Pallas at 128-lane-aligned channels, the
    fwd-only hybrid at other sublane-aligned counts, XLA for ragged
    channels or off-TPU; explicit on/off override both ways
    (receipts/micro_lrn.json)."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.delenv('CXXNET_PALLAS', raising=False)
    assert pk.pallas_mode() == 'auto'
    # off a real TPU (interpret mode) auto never turns pallas on
    monkeypatch.setattr(pk, '_interpret', lambda: True)
    assert pk.lrn_auto_mode(256) == 'xla'
    monkeypatch.setattr(pk, '_interpret', lambda: False)
    assert pk.lrn_auto_mode(256) == 'full'     # norm2: fwd+bwd 2.16x
    assert pk.lrn_auto_mode(96) == 'hybrid'    # norm1: fwd 1.90x, bwd loses
    assert pk.lrn_auto_mode(50) == 'xla'       # ragged channel count
    assert pk.lrn_auto_mode(24) == 'xla'       # below the measured floor
    monkeypatch.setenv('CXXNET_PALLAS', '0')
    assert pk.lrn_auto_mode(256) == 'xla'
    monkeypatch.setenv('CXXNET_PALLAS', '1')
    assert pk.lrn_auto_mode(96) == 'full'


def test_lrn_pallas_under_jit():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.rand(4, 2, 2, 16).astype(np.float32))
    f = jax.jit(lambda x: lrn_pallas(x, 5, 0.001, 0.75, 1.0))
    np.testing.assert_allclose(np.asarray(f(x)),
                               lrn_ref(np.asarray(x), 5, 0.001, 0.75, 1.0),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('m,k,n', [(100, 64, 70), (256, 512, 256)])
def test_pallas_matmul(m, k, n):
    rng = np.random.RandomState(3)
    a = rng.randn(m, k).astype(np.float32)
    b = rng.randn(k, n).astype(np.float32)
    out = np.asarray(pallas_matmul(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-4)


def test_lrn_layer_uses_pallas_when_enabled(monkeypatch):
    monkeypatch.setenv('CXXNET_PALLAS', '1')
    from cxxnet_tpu.layers import ForwardContext, NodeSpec, create_layer
    from cxxnet_tpu.layers.base import get_layer_type
    rng = np.random.RandomState(4)
    x = rng.rand(2, 3, 3, 8).astype(np.float32)
    layer = create_layer(get_layer_type('lrn'))
    layer.set_param('local_size', '5')
    layer.infer_shapes([NodeSpec(8, 3, 3)])
    ctx = ForwardContext(is_train=False)
    out = layer.forward({}, [jnp.asarray(x)], ctx)[0]
    np.testing.assert_allclose(np.asarray(out),
                               lrn_ref(x, 5, 0.001, 0.75, 1.0),
                               rtol=1e-5, atol=1e-6)


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


def test_lrn_pallas_rows_equal_channels():
    # regression: padded row count == channel count must not misroute the
    # band matrix (positional BlockSpec dispatch in _lrn_call)
    from cxxnet_tpu.ops import pallas_kernels as pk
    rng = np.random.RandomState(6)
    c = pk._ROW_TILE
    x = jnp.asarray(rng.rand(pk._ROW_TILE // 4, 2, 2, c).astype(np.float32))
    out = pk.lrn_pallas(x, 5, 0.001, 0.75, 1.0)
    np.testing.assert_allclose(np.asarray(out),
                               lrn_ref(np.asarray(x), 5, 0.001, 0.75, 1.0),
                               rtol=1e-4, atol=1e-5)


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


def test_lrn_auto_gate_scoped_to_single_device(monkeypatch):
    """The auto LRN hybrid must stand down inside multi-device GSPMD
    programs (no sharding rule for the opaque pallas_call); explicit
    use_pallas=1 still forces it.  The mesh size is threaded per-program
    through ForwardContext, not a process global."""
    from cxxnet_tpu.layers import ForwardContext
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.delenv('CXXNET_PALLAS', raising=False)
    monkeypatch.setattr(pk, '_interpret', lambda: False)
    assert pk.lrn_auto_mode(256, spmd_devices=1) == 'full'
    assert pk.lrn_auto_mode(256, spmd_devices=8) == 'xla'
    monkeypatch.setenv('CXXNET_PALLAS', '1')
    assert pk.lrn_auto_mode(256, spmd_devices=8) == 'full'
    assert ForwardContext(is_train=False).spmd_devices == 1


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
