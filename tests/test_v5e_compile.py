"""Compiles for a described v5e, no chip attached (the on-chip-measurement
guide's third rehearsal): the kernels of the main path at AlexNet's widths,
and that the names this repo gives them are the names the TPU's compiler
keeps.  All such compiles live in this one file: the worker that runs it
loads the TPU's library and holds it.  The topology is described inside a
fixture, never at import, and the tests skip where it cannot be."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cxxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope='module')
def one_chip():
    import os
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says "not here"
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # a compile for a described device is written to the persistent cache
    # and cannot be read back without a chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


def _custom_calls(hlo: str):
    """{instruction name: op_name} of the Mosaic custom calls."""
    return dict(re.findall(
        r'%([\w.]+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', hlo))


# AlexNet's two LRN layers (example/ImageNet/ImageNet.conf) at the published
# batch of 256: 27x27x96 takes the hybrid (Pallas forward, XLA backward),
# 13x13x256 the full Pallas pair (ops.pallas_kernels.lrn_auto_mode)
@pytest.mark.parametrize('shape,lrn,kernels', [
    ((256, 27, 27, 96), pk.lrn_hybrid, {'lrn_fwd'}),
    ((256, 13, 13, 256), pk.lrn_pallas, {'lrn_fwd', 'lrn_bwd'}),
])
def test_lrn_kernels_keep_their_names_on_the_v5e(one_chip, monkeypatch,
                                                 shape, lrn, kernels):
    monkeypatch.setattr(pk, '_interpret', lambda: False)

    def loss(x):
        with jax.named_scope('l03_lrn'):
            y = lrn(x, 5, 1e-4, 0.75, 1.0)
        return jnp.sum(y.astype(jnp.float32))

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    hlo = jax.jit(jax.grad(loss)).lower(x).compile().as_text()
    calls = _custom_calls(hlo)
    assert {re.sub(r'[.\d]+$', '', n) for n in calls} == kernels
    for name, op_name in calls.items():
        want = ('transpose(jvp(l03_lrn))' if name.startswith('lrn_bwd')
                else 'jvp(l03_lrn)')
        assert want in op_name and name.split('.')[0] in op_name
