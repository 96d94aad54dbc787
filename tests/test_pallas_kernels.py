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
